"""Multi-chip dryrun at larger/uneven device counts (VERDICT r3 #10).

Runs __graft_entry__.dryrun_multichip in subprocesses with N virtual CPU
devices: 16 (the next pod step beyond the driver's 8-device check) and 12
(uneven — a non-power-of-two mesh forces factorizations like dp=2,tp=2,pp=3
and sp=2,ep=6 through every sharding rule). Both passes must execute and
print finite losses.
"""

import os
import re
import subprocess
import sys

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_ENTRY = os.path.join(_REPO, "__graft_entry__.py")


def _run(n_devices: int) -> str:
    env = {
        **os.environ,
        "JAX_PLATFORMS": "cpu",
        "RAY_TPU_NUM_TPUS": "0",
        "XLA_FLAGS": f"--xla_force_host_platform_device_count={n_devices}",
    }
    proc = subprocess.run(
        [sys.executable, _ENTRY, str(n_devices)],
        capture_output=True,
        text=True,
        timeout=900,
        env=env,
        cwd=_REPO,
    )
    assert proc.returncode == 0, (
        f"dryrun_multichip({n_devices}) failed:\n{proc.stdout[-2000:]}\n{proc.stderr[-3000:]}"
    )
    return proc.stdout


@pytest.mark.parametrize("n_devices", [16, 12])
def test_dryrun_multichip_scales(n_devices):
    out = _run(n_devices)
    m = re.search(
        rf"dryrun_multichip\({n_devices}\): pass1\(dp=(\d+),tp=(\d+),pp=(\d+)\) "
        r"loss=([\d.]+); pass2\(sp=(\d+),ep=(\d+),moe\) loss=([\d.]+)",
        out,
    )
    assert m, f"unexpected dryrun output:\n{out[-1500:]}"
    dp, tp, pp, loss1, sp, ep, loss2 = m.groups()
    assert int(dp) * int(tp) * int(pp) == n_devices
    assert int(sp) * int(ep) == n_devices
    if n_devices == 12:
        # Uneven: at least one factor is not a power of two.
        assert any(int(x) % 2 == 1 and int(x) > 1 for x in (dp, tp, pp, sp, ep))
    assert float(loss1) == float(loss1) and float(loss1) < 100  # finite, sane
    assert float(loss2) == float(loss2) and float(loss2) < 100
