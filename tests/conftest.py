"""Test configuration: force an 8-device virtual CPU mesh before JAX imports.

All engine/parallel tests run on a CPU-emulated 8-device mesh so that
tp/dp/sp/ep shardings are exercised hermetically (no TPU needed), mirroring
how the driver dry-runs `__graft_entry__.dryrun_multichip`.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ.setdefault("TPU_STACK_LOG_LEVEL", "WARNING")

import asyncio  # noqa: E402
import fcntl  # noqa: E402
import inspect  # noqa: E402
import shutil  # noqa: E402
import subprocess  # noqa: E402

import pytest  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NATIVE_BUILD_DIR = os.path.join(REPO, "native", "build")


@pytest.fixture(scope="session")
def native_build():
    """native/build, built from the committed sources (it is git-ignored,
    so a clean checkout has none). Every xdist worker that needs it calls
    this; a file lock lets one build while the others wait, and the
    build tools make the later calls no-ops. Skips only where there is
    no C++ compiler."""
    cxx = shutil.which("c++") or shutil.which("g++") or shutil.which(
        "clang++")
    if cxx is None:
        pytest.skip("no C++ compiler")
    src = os.path.join(REPO, "native")
    os.makedirs(NATIVE_BUILD_DIR, exist_ok=True)
    with open(os.path.join(NATIVE_BUILD_DIR, ".lock"), "w") as lock:
        fcntl.flock(lock, fcntl.LOCK_EX)
        if shutil.which("cmake"):
            commands = [
                ["cmake", "-S", src, "-B", NATIVE_BUILD_DIR, "-G",
                 "Ninja" if shutil.which("ninja") else "Unix Makefiles"],
                ["cmake", "--build", NATIVE_BUILD_DIR]]
        elif os.path.exists(os.path.join(NATIVE_BUILD_DIR,
                                         "tpu-stack-h2fuzz")):
            commands = []  # built by an earlier worker of this run
        else:
            # native/CMakeLists.txt by hand: the library, then the
            # binaries (h2fuzz last: it marks the build complete).
            flags = [cxx, "-std=c++17", "-O2", "-Wall", "-Wextra"]
            lib = ["-L", NATIVE_BUILD_DIR, "-ltpu_stack_pickers",
                   "-Wl,-rpath,$ORIGIN"]
            out = lambda name: ["-o", os.path.join(NATIVE_BUILD_DIR, name)]  # noqa: E731
            commands = [
                flags + ["-shared", "-fPIC", f"{src}/pickers/pickers.cc"]
                + out("libtpu_stack_pickers.so") + ["-lpthread"],
                flags + [f"{src}/operator/operator.cc"]
                + out("tpu-stack-operator") + ["-lpthread"],
                flags + [f"{src}/epp/epp_server.cc"] + out("tpu-stack-epp")
                + lib + ["-lpthread"],
                flags + [f"{src}/epp/epp_bench.cc"]
                + out("tpu-stack-epp-bench") + ["-lpthread"],
                flags + [f"{src}/epp/h2fuzz.cc"] + out("tpu-stack-h2fuzz")
                + lib + ["-lpthread"]]
        for command in commands:
            subprocess.run(command, check=True, capture_output=True)
    os.environ["TPU_STACK_NATIVE_LIB"] = NATIVE_BUILD_DIR
    # Force a re-probe: the library may have been looked for before it
    # was built.
    import production_stack_tpu.native as native

    native._load_attempted = False
    native._lib = None
    assert native.available()
    return NATIVE_BUILD_DIR


def pytest_collection_modifyitems(items):
    for item in items:
        if inspect.iscoroutinefunction(getattr(item, "function", None)):
            item.add_marker(pytest.mark.asyncio)


@pytest.hookimpl(tryfirst=True)
def pytest_pyfunc_call(pyfuncitem):
    """Minimal asyncio test support (pytest-asyncio may not be installed)."""
    func = pyfuncitem.function
    if inspect.iscoroutinefunction(func):
        sig = inspect.signature(func)
        kwargs = {
            name: pyfuncitem.funcargs[name]
            for name in sig.parameters
            if name in pyfuncitem.funcargs
        }
        asyncio.run(func(**kwargs))
        return True
    return None

# The KV device pipe (jax.experimental.transfer) probes availability in a
# pair of child processes on first use; tests run against the HTTP relay by
# default and exercise the device path through a fake pipe
# (test_kv_device_pipe).
os.environ.setdefault("TPU_STACK_KV_DEVICE_PIPE", "0")
