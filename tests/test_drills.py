"""The four behaviour drills (``tests/drills.py``), one case a leg.

Each drill runs ONCE a module, in a process of its own on two simulated
CPU devices; every case reads the verdict of one leg, so a red leg names
itself and the green ones go on guarding what they guard."""

import functools
import json
import os
import subprocess
import sys

import pytest

DRILLS_PY = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         "drills.py")

LEGS = [
    ("serving", "continuous_beats_static"), ("serving", "prefix_sharing"),
    ("serving", "speculative"), ("serving", "chunked_prefill"),
    ("serving", "quantization"), ("serving", "retention"), ("serving", "tp"),
    ("faults", "crash"), ("faults", "corrupt"), ("faults", "preempt"),
    ("fleet", "inprocess"), ("fleet", "process"), ("fleet", "tracing"),
    ("fleet", "disagg"), ("fleet", "chaos"),
    ("spawn", "warm_start"),
]


def two_cpu_devices(env):
    """A copy of ``env`` pinned to a virtual two-device CPU platform (it
    must land before the child's jax initializes); any device-count flag
    already there (``conftest.py`` forces eight) is scrubbed first."""
    flags = [f for f in env.get("XLA_FLAGS", "").split()
             if "xla_force_host_platform_device_count" not in f]
    flags.append("--xla_force_host_platform_device_count=2")
    return dict(env, JAX_PLATFORMS="cpu", XLA_FLAGS=" ".join(flags))


@functools.cache
def verdict(drill):
    """The drill's last output line, parsed; the stderr tail in its place
    when the process printed none. Cached: one run a drill a module."""
    res = subprocess.run(
        [sys.executable, DRILLS_PY, drill], capture_output=True, text=True,
        timeout=600, env=two_cpu_devices(os.environ))
    try:
        return json.loads(res.stdout.strip().splitlines()[-1])
    except (ValueError, IndexError):
        return {"error": f"rc {res.returncode}, no verdict on stdout; "
                         f"stderr: {res.stderr[-2000:]}"}


@pytest.mark.parametrize("drill,leg", LEGS,
                         ids=[f"{d}-{leg}" for d, leg in LEGS])
def test_drill_leg(drill, leg):
    out = verdict(drill)
    assert "error" not in out, out["error"]
    assert out[leg]["ok"] is True, json.dumps(out[leg], indent=1)
