"""Test harness: simulate a multi-chip mesh with 8 virtual CPU devices.

This replaces the reference's ``mpirun -np N`` harness (SURVEY §4): tier-a
pure-logic tests need no devices, tier-b "world of 1" tests run the full
worker→dispatcher→table path in-process, tier-c multi-shard tests run on the
8-device virtual mesh.

Sanitizer env hooks (``docs/static_analysis.md``):

- ``MV_LOCKCHECK=1`` — wrap the threading lock factories *before* the
  package imports (fault/lockcheck.py); any test whose run records a
  lock-order cycle or a hold-time outlier fails with the report, and a
  session summary lands in ``MV_CHAOS_ARTIFACT_DIR`` when set.
- ``MV_STRICT=1`` — silent thread death (an uncaught exception in any
  ``threading.Thread``) fails the test that produced it, and
  ``ResourceWarning`` (leaked sockets/rings/files) becomes an error.
- ``faulthandler`` is always on with a watchdog timer: a test wedged
  past ~2/3 of the suite timeout dumps every thread's stack to stderr,
  so a CI hang ships the evidence instead of a bare SIGKILL.
"""

import faulthandler
import fcntl
import os
import subprocess
import threading
import warnings

# Must be set before jax initializes its backends. Force CPU even when the
# ambient environment points at a TPU platform: tests simulate a multi-chip
# mesh with 8 virtual CPU devices.
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

MV_LOCKCHECK = os.environ.get("MV_LOCKCHECK", "") == "1"
MV_STRICT = os.environ.get("MV_STRICT", "") == "1"

if MV_LOCKCHECK:
    # Patch the lock factories before multiverso_tpu imports so every
    # lock the package creates (module-level registries included) is
    # order-checked.
    from multiverso_tpu.fault import lockcheck
    lockcheck.enable()

import pytest  # noqa: E402

import multiverso_tpu as mv  # noqa: E402
from multiverso_tpu.config import FLAGS  # noqa: E402
from multiverso_tpu.dashboard import Dashboard  # noqa: E402
from multiverso_tpu.runtime.zoo import Zoo  # noqa: E402

# Dump all thread stacks if the whole run wedges (the per-suite timeout
# is 870s in ROADMAP's tier-1 command; dump well before the outer
# timeout -k fires so the evidence beats the SIGKILL).
faulthandler.enable()
faulthandler.dump_traceback_later(600, repeat=True, exit=False)

# Record uncaught exceptions from worker threads; a thread dying silently
# is a bug even when the test's assertions happen to pass.
_thread_deaths = []
_orig_excepthook = threading.excepthook


def _recording_excepthook(args):
    _thread_deaths.append("thread %r died: %s: %s" % (
        args.thread.name if args.thread else "?",
        getattr(args.exc_type, "__name__", args.exc_type), args.exc_value))
    _orig_excepthook(args)


threading.excepthook = _recording_excepthook


def _apply_env_flag_overrides():
    """CI chaos-matrix hook: MV_WIRE_COALESCE_FRAMES/_BYTES force the
    vectored-send caps, MV_WIRE_SHM=1 forces the shared-memory ring
    transport, and MV_APPLY_BATCH_MSGS overrides the dispatcher's fused-
    apply cap — so fault injection exercises a chosen wire/apply posture
    for a whole suite run (ci.yml matrix entries set them)."""
    for env, flag in (("MV_WIRE_COALESCE_FRAMES", "wire_coalesce_frames"),
                      ("MV_WIRE_COALESCE_BYTES", "wire_coalesce_bytes"),
                      ("MV_WIRE_SHM", "wire_shm"),
                      ("MV_APPLY_BATCH_MSGS", "apply_batch_msgs"),
                      ("MV_READ_PREFERENCE", "read_preference"),
                      ("MV_CLIENT_CACHE_BYTES", "client_cache_bytes")):
        raw = os.environ.get(env)
        if raw:
            mv.set_flag(flag, raw)


@pytest.fixture(autouse=True)
def clean_runtime():
    """Reference's MultiversoEnv fixture: fresh flags + runtime per test."""
    FLAGS.reset()
    _apply_env_flag_overrides()
    Dashboard.reset()
    yield
    try:
        if Zoo.instance().started:
            mv.shutdown()
    finally:
        Zoo._reset_instance()
        FLAGS.reset()


@pytest.fixture(autouse=True)
def _sanitizers(request):
    """Per-test sanitizer verdicts: lockcheck findings and (under
    MV_STRICT=1) silent thread deaths fail the test that produced them."""
    deaths_before = len(_thread_deaths)
    if MV_STRICT:
        with warnings.catch_warnings():
            warnings.simplefilter("error", ResourceWarning)
            yield
    else:
        yield
    failures = []
    if MV_LOCKCHECK:
        from multiverso_tpu.fault import lockcheck
        if lockcheck.findings():
            failures.append("lockcheck:\n" + lockcheck.report_text())
            lockcheck.take_findings()
    if MV_STRICT and len(_thread_deaths) > deaths_before:
        failures.append("silent thread death(s):\n  " +
                        "\n  ".join(_thread_deaths[deaths_before:]))
    if failures:
        pytest.fail("\n\n".join(failures), pytrace=False)


def pytest_sessionfinish(session, exitstatus):
    """Ship the lockcheck session summary with the chaos artifacts."""
    if not MV_LOCKCHECK:
        return
    art_dir = os.environ.get("MV_CHAOS_ARTIFACT_DIR")
    if not art_dir:
        return
    from multiverso_tpu.fault import lockcheck
    try:
        os.makedirs(art_dir, exist_ok=True)
        path = os.path.join(art_dir, "lockcheck-report.txt")
        with open(path, "w", encoding="utf-8") as fp:
            text = lockcheck.report_text()
            fp.write(text if text else
                     "lockcheck: no lock-order cycles or hold-time "
                     "outliers recorded this session\n")
    except OSError:
        pass


NATIVE_DIR = os.path.join(os.path.dirname(__file__), "..", "multiverso_tpu",
                          "native")
NATIVE_LIB = os.path.join(NATIVE_DIR, "libmultiverso_tpu.so")


def _make_native(*targets):
    with open(os.path.join(NATIVE_DIR, "Makefile")) as makefile:
        fcntl.flock(makefile, fcntl.LOCK_EX)
        subprocess.run(["make", "-C", NATIVE_DIR, *targets], check=True,
                       capture_output=True)


@pytest.fixture(scope="session")
def make_native():
    """``make <targets>`` in the native directory, one at a time on this
    machine: the suite's workers are processes, and two makes at once write
    the same object files. The lock is on the Makefile itself (no file is
    added to the tree)."""
    return _make_native


@pytest.fixture(scope="session")
def native_lib(make_native):
    """The built native library's path. Every test that needs the library
    asks here, so that none depends on which worker built it first (a clean
    tree has none). ``make`` is incremental: a built library costs a
    ``stat`` a source. The codec's loader caches a failed load, and code
    that ran in this worker before the build may have asked: it is made to
    ask again, as it would find the library on a tree built beforehand."""
    from multiverso_tpu.utils import quantization
    make_native()
    if quantization._native is None:
        quantization._native_load_attempted = False
    return NATIVE_LIB


@pytest.fixture
def mv_env():
    """World-of-1 environment: this process is worker 0 and all server shards."""
    mv.init()
    yield
    mv.shutdown()


@pytest.fixture
def sync_env():
    mv.init(sync=True)
    yield
    mv.shutdown()
