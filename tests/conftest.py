"""Test configuration.

Forces JAX onto a virtual 8-device CPU platform *before* jax is imported
anywhere, so sharding/mesh tests exercise real multi-device SPMD without TPU
hardware (the strategy the task mandates; the reference instead reran its
suite under `mpirun -np 2`, /root/reference/.travis.yml:96-103 -- our
equivalent lives in tests/distributed.py, which respawns ranks as processes).
"""

import contextlib
import faulthandler
import os
import signal
import sys
import threading

# Unconditional: the ambient environment may point JAX at a real TPU; the
# test suite always runs on the virtual CPU mesh.
os.environ["JAX_PLATFORMS"] = "cpu"
# Env vars inherited by the rank subprocesses of tests/distributed.py.
os.environ.pop("TPU_WORKER_HOSTNAMES", None)
os.environ.pop("TPU_WORKER_ID", None)
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    _flags += " --xla_force_host_platform_device_count=8"
# Most of a test's seconds are XLA's CPU backend compiling a program that
# runs once: without LLVM's optimisation passes a compile takes half the CPU
# time.  The values move in a float's last bits (LLVM vectorises no sum at
# level 0), inside every tolerance of the suite; the one place that pins bits,
# tests/test_flash_table.py's digests, asks a process at the default level.
# libtpu does not read the option: a described chip's program is the same
# text at every level.
if "xla_backend_optimization_level" not in _flags:
    _flags += " --xla_backend_optimization_level=0"
os.environ["XLA_FLAGS"] = _flags.strip()
# The persistent compilation cache stays off under test, in this process
# and in every rank and example it starts (common/compile_cache.py places
# the cache; this switch is JAX's own).
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"
os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)
# libtpu's own name: several processes may load it at once.  The `v5e`
# fixture below describes a topology in every xdist worker that runs a file
# of described-chip compiles; without this the second one aborts on libtpu's
# lock file ("Internal error when accessing libtpu multi-process lockfile").
os.environ["ALLOW_MULTIPLE_LIBTPU_LOAD"] = "1"
# Keep XLA's CPU threadpools small: tests run many processes.
os.environ.setdefault("XLA_CPU_MULTI_THREAD_EIGEN", "false")

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import pytest  # noqa: E402


# Seconds a test's set-up, and its call, may each take (the heaviest case is
# under 90 s on a loaded machine; a multi-rank test's own limit is 180 s).
TEST_LIMIT_S = 300
_stderr_fd = None       # the terminal's, duplicated before capture takes fd 2


def pytest_configure(config):
    global _stderr_fd
    if _stderr_fd is None:
        _stderr_fd = os.dup(2)
    config.addinivalue_line(
        "markers",
        "quick: in-process tests (no rank subprocesses); `-m quick` is the "
        "fast PR-iteration tier (<3 min), `-m 'not quick'` the distributed "
        "tier.")
    config.addinivalue_line(
        "markers",
        "slow: multi-rank system tests excluded from the tier-1 budget "
        "(`-m 'not slow'`); run them explicitly with `-m slow`.")


def pytest_collection_modifyitems(config, items):
    """Auto-tier: tests decorated with @distributed_test spawn fresh rank
    processes, and the example system tests spawn multi-rank training
    subprocesses (with framework deps the quick CI job doesn't install);
    everything else runs in-process and forms the quick tier."""
    for item in items:
        if item.fspath.basename == "test_examples.py":
            continue
        fn = getattr(item, "function", None)
        if (fn is not None and not hasattr(fn, "__wrapped_rank_fn__")
                and item.get_closest_marker("slow") is None):
            item.add_marker(pytest.mark.quick)


@contextlib.contextmanager
def _limited(item):
    """A limit of `TEST_LIMIT_S` on what runs inside: past it every thread's
    stack goes to stderr and the test fails by name, so that a run the
    driver's clock cuts says where it was.  A wait in Python is interrupted
    by the alarm; a wait inside one C++ call is not, and the watchdog thread
    prints the stacks a second later all the same."""
    if not hasattr(signal, "SIGALRM") \
            or threading.current_thread() is not threading.main_thread():
        yield
        return

    def past_limit(signum, frame):
        faulthandler.cancel_dump_traceback_later()
        faulthandler.dump_traceback(file=_stderr_fd, all_threads=True)
        pytest.fail(f"{item.nodeid} ran past its {TEST_LIMIT_S} s limit",
                    pytrace=False)

    before = signal.signal(signal.SIGALRM, past_limit)
    faulthandler.dump_traceback_later(TEST_LIMIT_S + 1, file=_stderr_fd)
    signal.alarm(TEST_LIMIT_S)
    try:
        yield
    finally:
        signal.alarm(0)
        faulthandler.cancel_dump_traceback_later()
        signal.signal(signal.SIGALRM, before)


@pytest.hookimpl(wrapper=True)
def pytest_runtest_setup(item):
    with _limited(item):
        return (yield)


@pytest.hookimpl(wrapper=True)
def pytest_runtest_call(item):
    with _limited(item):
        return (yield)


@pytest.fixture(scope="session")
def v5e():
    """The devices of a described (not attached) v5e 2x2 host, for libtpu to
    compile for (tests/test_chip_kernels.py, tests/test_chip_steps.py); skips
    where libtpu cannot describe one."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as exc:  # no libtpu in this installation
        pytest.skip(f"cannot describe a v5e:2x2 topology: {exc}")
    return topo.devices


@pytest.fixture
def single_process_hvd():
    """hvd.init() at size 1 (no env), shut down afterwards."""
    for var in ("HVD_TPU_RANK", "HVD_TPU_SIZE", "HVD_TPU_COORD",
                "HVD_TPU_DATA"):
        os.environ.pop(var, None)
    import horovod_tpu as hvd

    hvd.init()
    yield hvd
    hvd.shutdown()
