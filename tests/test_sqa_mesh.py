"""`SQAJob` on a slot pool sharded over four devices: the checks of
tests/test_sqa.py (served equals solo replay, snapshot mid-ramp) run in a
subprocess that forces four CPU devices, so they run on any host; a
snapshot taken on four devices restores onto one, and the reverse."""

import os
import subprocess
import sys

import pytest

import repro

_SRC = os.path.abspath(os.path.join(list(repro.__path__)[0], ".."))
_HERE = os.path.dirname(os.path.abspath(__file__))
CASES = [("jnp", "a4"), ("jnp", "cb"), ("pallas", "a4"), ("pallas", "cb")]


def _four_devices(code: str) -> None:
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([_SRC, _HERE, os.environ.get("PYTHONPATH", "")]))
    out = subprocess.run([sys.executable, "-c", code], env=env, capture_output=True,
                         text=True, timeout=600)
    assert out.returncode == 0, out.stdout[-3000:] + out.stderr[-6000:]
    assert "four-devices-ok" in out.stdout


@pytest.mark.parametrize("backend,rung", CASES)
def test_served_sqa_job_equals_solo_replay_on_four_devices(backend, rung):
    _four_devices(
        "import jax\n"
        "assert len(jax.devices()) == 4\n"
        "from repro.launch.mesh import make_slot_mesh\n"
        "import test_sqa\n"
        f"test_sqa.served_equals_solo({backend!r}, {rung!r}, mesh=make_slot_mesh(4))\n"
        "print('four-devices-ok')\n"
    )


@pytest.mark.parametrize("rung", ["a4", "cb"])
def test_snapshot_mid_ramp_restores_across_device_counts(tmp_path, rung):
    _four_devices(
        "from repro.launch.mesh import make_slot_mesh\n"
        "import test_sqa\n"
        f"test_sqa.snapshot_resumes('jnp', {rung!r}, {str(tmp_path / 'a')!r}, "
        "mesh=make_slot_mesh(4), restore_mesh=None)\n"
        f"test_sqa.snapshot_resumes('jnp', {rung!r}, {str(tmp_path / 'b')!r}, "
        "mesh=None, restore_mesh=make_slot_mesh(4))\n"
        "print('four-devices-ok')\n"
    )
