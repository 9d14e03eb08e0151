"""Driver entry-point contract: dryrun_multichip must be self-sufficient.

Round-1 regression: the calling interpreter saw a single device, and
``dryrun_multichip(8)`` crashed instead of provisioning its own virtual mesh.
The wrapper must fall back to a subprocess with a forced
``--xla_force_host_platform_device_count`` CPU mesh whenever the caller has
fewer devices than requested.
"""

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import __graft_entry__ as graft  # noqa: E402


def test_dryrun_subprocess_fallback_when_too_few_devices(monkeypatch):
    calls = {}

    monkeypatch.setattr(
        graft, "_dryrun_in_subprocess",
        lambda n: calls.setdefault("sub", n),
    )
    monkeypatch.setattr(
        graft, "_dryrun_impl",
        lambda n: calls.setdefault("impl", n),
    )

    # More devices than this interpreter has -> subprocess path.
    huge = 10_000
    graft.dryrun_multichip(huge)
    assert calls == {"sub": huge}

    # Enough devices (the conftest forces an 8-device CPU mesh) -> in-process.
    calls.clear()
    graft.dryrun_multichip(8)
    assert calls == {"impl": 8}


def test_subprocess_env_forces_cpu_mesh(monkeypatch):
    """The re-exec must force JAX_PLATFORMS=cpu and the device-count flag."""
    captured = {}

    def fake_run(cmd, **kwargs):
        captured["cmd"] = cmd
        captured["env"] = kwargs["env"]

        class R:
            returncode = 0
            stdout = stderr = ""

        return R()

    monkeypatch.setattr(graft.subprocess, "run", fake_run)
    graft._dryrun_in_subprocess(8)

    env = captured["env"]
    assert env["JAX_PLATFORMS"] == "cpu"
    assert "--xla_force_host_platform_device_count=8" in env["XLA_FLAGS"]
    # A stale forced count from the parent env must not linger.
    assert env["XLA_FLAGS"].count("xla_force_host_platform_device_count") == 1
    assert captured["cmd"][0] == sys.executable
