"""Test harness config.

Tests run on a simulated 8-device CPU mesh
(--xla_force_host_platform_device_count=8, the JAX analogue of the
reference's in-process multi-GPU/pserver tests — SURVEY.md §4.5) so
multi-chip sharding is exercised without TPU hardware. chipbench/ and
chip_smoke.py do NOT import this and use the real TPU. The persistent
compile cache (paddle_tpu/compile_cache.py) is not enabled here.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
xla_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in xla_flags:
    os.environ["XLA_FLAGS"] = (
        xla_flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

# full-f32 matmul/conv numerics for the oracle comparisons (XLA CPU's
# default conv precision is reduced — SURVEY.md §7 hard part 7: keep a
# faithful CPU reference path for tests)
jax.config.update("jax_default_matmul_precision", "highest")

import faulthandler  # noqa: E402
import functools  # noqa: E402

import pytest  # noqa: E402

# ---------------------------------------------------------------------------
# Environment capability probes (probed ONCE here; tests opt in via the
# `needs_cpu_multiprocess` / `needs_multidevice_pp` markers and are
# reported as environment SKIPS — not failures — where the capability is
# absent.)
# ---------------------------------------------------------------------------

# the pipeline executor's mesh mode places stages on a pp mesh axis —
# meaningless (and unconstructible: dp*pp > devices) with one device.
# Probed once at import; on the simulated 8-device
# CPU mesh above this is True, on a 1-device CI host the marked tests
# become environment skips.
HAS_MULTIDEVICE_PP = len(jax.devices()) >= 2

_MP_PROBE_CHILD = r"""
import os
import numpy as np
import jax
jax.config.update("jax_platforms", "cpu")
jax.distributed.initialize(
    coordinator_address=os.environ["PT_PROBE_COORD"],
    num_processes=2, process_id=int(os.environ["PT_PROBE_PID"]))
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
devs = jax.devices()
assert len(devs) == 2, devs
mesh = Mesh(np.array(devs), ("dp",))
sh = NamedSharding(mesh, P("dp"))
x = jax.make_array_from_process_local_data(sh, np.ones((1,), np.float32))
s = jax.jit(lambda a: jnp.sum(a))(x)  # needs a cross-process collective
assert float(s) == 2.0, s
print("probe ok", flush=True)
"""


@functools.lru_cache(maxsize=1)
def cpu_multiprocess_ok() -> bool:
    """Can two localhost CPU processes form a jax.distributed pair and
    run one cross-process collective? This jaxlib's CPU backend raises
    'Multiprocess computations aren't implemented' at dispatch, which
    is only observable by actually doing it — so the probe is a minimal
    2-process psum, run at most once per session (lru_cache) and only
    when a `needs_cpu_multiprocess` test was collected."""
    import socket
    import subprocess
    import sys

    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    procs = []
    for pid in range(2):
        env = dict(os.environ)
        env.update(PT_PROBE_COORD=f"127.0.0.1:{port}",
                   PT_PROBE_PID=str(pid), JAX_PLATFORMS="cpu",
                   XLA_FLAGS="--xla_force_host_platform_device_count=1")
        env.pop("JAX_NUM_CPU_DEVICES", None)
        procs.append(subprocess.Popen(
            [sys.executable, "-c", _MP_PROBE_CHILD], env=env,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True))
    ok = True
    for p in procs:
        try:
            p.communicate(timeout=120)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
                q.communicate()
            return False
        ok = ok and p.returncode == 0
    return ok


# Session time budget for the `fleet` marker: multi-process fleet tests
# are individually bounded, but a pathological environment (slow model
# loads, starved CPU) can make the WHOLE family eat the tier-1 timeout.
# Once the cumulative call-phase time of fleet-marked tests crosses the
# budget, the remaining ones SKIP loudly instead of letting `timeout -k`
# kill the run with no diagnosis. PT_FLEET_TEST_BUDGET_S=0 disables.
_FLEET_BUDGET_S = float(os.environ.get("PT_FLEET_TEST_BUDGET_S", "420"))
_fleet_spent = {"s": 0.0}


def pytest_runtest_setup(item):
    if (_FLEET_BUDGET_S > 0 and item.get_closest_marker("fleet")
            and _fleet_spent["s"] >= _FLEET_BUDGET_S):
        pytest.skip(
            f"fleet test time budget exhausted "
            f"({_fleet_spent['s']:.0f}s spent >= {_FLEET_BUDGET_S:.0f}s; "
            "raise PT_FLEET_TEST_BUDGET_S to run everything)")


def pytest_runtest_logreport(report):
    if report.when == "call" and "fleet" in report.keywords:
        _fleet_spent["s"] += report.duration


def pytest_collection_modifyitems(config, items):
    need_mp = [it for it in items
               if it.get_closest_marker("needs_cpu_multiprocess")]
    mp_ok = cpu_multiprocess_ok() if need_mp else True
    skip_mp = pytest.mark.skip(
        reason="environment: this jaxlib's CPU backend does not "
               "implement multiprocess computations (probed once by "
               "conftest.cpu_multiprocess_ok)")
    skip_pp = pytest.mark.skip(
        reason="environment: a single-device backend cannot place "
               "pipeline stages on a pp mesh axis")
    for it in items:
        if not mp_ok and it.get_closest_marker("needs_cpu_multiprocess"):
            it.add_marker(skip_mp)
        if (not HAS_MULTIDEVICE_PP
                and it.get_closest_marker("needs_multidevice_pp")):
            it.add_marker(skip_pp)


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "needs_cpu_multiprocess: requires multiprocess computations on "
        "the CPU backend (2-process jax.distributed collectives); "
        "probed once per session, skipped where unimplemented")
    config.addinivalue_line(
        "markers",
        "needs_multidevice_pp: requires >= 2 devices to place pipeline "
        "stages on a pp mesh axis; skipped (environment, not failure) "
        "on single-device backends")
    config.addinivalue_line(
        "markers",
        "slow: long-running; excluded from the tier-1 run (-m 'not slow')")
    config.addinivalue_line(
        "markers",
        "chaos: fault-injection / crash-recovery tests "
        "(paddle_tpu.resilience); the fast deterministic subset runs in "
        "tier-1, subprocess e2e cases are additionally marked slow")
    config.addinivalue_line(
        "markers",
        "fleet: multi-process router/fleet e2e tests "
        "(paddle_tpu.serving.router) that SPAWN replica subprocesses; "
        "in tier-1 but individually time-bounded like test_chaos, and "
        "collectively bounded by the PT_FLEET_TEST_BUDGET_S session "
        "budget (conftest.pytest_runtest_setup)")
    # hung multi-process / subprocess tests must leave a diagnosis: dump
    # every thread's traceback shortly before the tier-1 `timeout -k`
    # wrapper would SIGKILL the run (and again every interval for longer
    # local runs). PT_TEST_FAULTHANDLER_TIMEOUT=0 disables.
    faulthandler.enable()
    dump_after = float(os.environ.get("PT_TEST_FAULTHANDLER_TIMEOUT", "840"))
    if dump_after > 0:
        faulthandler.dump_traceback_later(dump_after, repeat=True)


def pytest_unconfigure(config):
    faulthandler.cancel_dump_traceback_later()


@pytest.fixture(autouse=True)
def fresh_state():
    import paddle_tpu as pt

    pt.reset()
    yield


@pytest.fixture(params=["sync", "async"])
def sync_mode(request):
    """Parametrize a trainer test over both host-sync modes of the
    pipelined step loop without duplicating the body: "sync" forces the
    legacy per-step readback (sync_every=1), "async" a coarse cadence so
    the on-device accumulator / lazy-cost path is what actually runs.
    The two must be observably identical — that equivalence IS the
    contract the parametrization enforces across tier-1."""
    from paddle_tpu.flags import FLAGS

    saved = FLAGS.sync_every
    FLAGS.sync_every = 1 if request.param == "sync" else 64
    yield request.param
    FLAGS.sync_every = saved


@pytest.fixture(params=["step", "async", "scan"])
def windowed(request):
    """sync_mode extended with the ISSUE 6 scan-window mode: "step" is
    the per-step-sync legacy loop, "async" the cadence-sync pipelined
    loop, "scan" fuses 4 steps per compiled lax.scan window. A trainer
    test taking this fixture runs in all three — the three loops must be
    observably identical (same convergence, same resume positions up to
    window quantization), which keeps the step/async/scan matrix green
    by construction as the trainer grows."""
    from paddle_tpu.flags import FLAGS

    saved = (FLAGS.sync_every, FLAGS.scan_window)
    FLAGS.sync_every, FLAGS.scan_window = {
        "step": (1, 0),
        "async": (64, 0),
        "scan": (64, 4),
    }[request.param]
    yield request.param
    FLAGS.sync_every, FLAGS.scan_window = saved
