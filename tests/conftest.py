"""Test harness: force an 8-device virtual CPU mesh.

The reference's distributed unit tests multiplex one host into N ranks via a
process pool (``tests/unit/common.py DistributedTest``).  The JAX-native
equivalent needs no processes at all: ``--xla_force_host_platform_device_count``
gives N virtual CPU devices in-process, and every multi-chip code path
(shard_map, collectives, GSPMD) runs against them unchanged.

The platform is pinned through ``jax.config`` so the suite runs on the CPU
whatever ``JAX_PLATFORMS`` says; nothing here loads the TPU library.
"""

import os

# Must be in place before the XLA CPU client initializes.
flags = os.environ.get("XLA_FLAGS", "")
if "--xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()
os.environ.setdefault("DSTPU_ACCELERATOR", "cpu")

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402


@pytest.fixture(scope="session")
def devices():
    devs = jax.devices()
    assert len(devs) == 8, f"expected 8 virtual devices, got {len(devs)}"
    return devs


@pytest.fixture(autouse=True)
def _reset_topology():
    yield
    from deepspeed_tpu.parallel import topology

    topology.reset_topology()


@pytest.fixture(autouse=True, scope="module")
def _release_compiled_programs():
    """A compiled CPU program keeps its code mapped: some hundreds of memory
    maps each for a step program that holds an interpret-mode Pallas kernel
    (130-400 a kernel), and a process may hold ``vm.max_map_count`` (65,530)
    of them; past that the next compile dies of a segmentation fault, in
    whatever test happens to run then.  So a worker that has gathered half of
    that drops the compiled programs of the modules it has finished."""
    yield
    try:
        with open("/proc/self/maps") as f:
            held = sum(1 for _ in f)
    except OSError:  # no procfs: nothing to count
        return
    if held > 30_000:
        jax.clear_caches()


def pytest_sessionfinish(session, exitstatus):
    """Lockdep gate (ISSUE 17): under ``DSTPU_LOCKDEP=1`` every suite in
    this pytest process ran with named-lock order tracking; assert the
    accumulated report empty modulo ``analysis/waivers.toml`` and print
    the one-line summary t1.sh aggregates next to DOTS_PASSED.  Runs
    after capture teardown, so the output always reaches the log."""
    from deepspeed_tpu.utils import locks

    if not locks.lockdep_enabled():
        return
    from deepspeed_tpu.analysis import concurrency

    report = locks.lockdep_report()
    try:
        waivers = concurrency.load_waivers()
    except Exception as e:  # noqa: BLE001 — a bad waiver file must fail
        # the run loudly, not crash the hook half-printed
        print(f"\nLOCKDEP WAIVER FILE INVALID: {e}")
        session.exitstatus = 1
        return
    split = concurrency.apply_waivers(report, waivers)
    print("\n" + concurrency.summary_line(report, len(split["waived"])))
    for key in split["unused_waivers"]:
        # not an error: partitioned tier-1 groups don't all exercise
        # every waived path
        print(f"LOCKDEP note: waiver unused in this session: {key}")
    if split["unwaived"]:
        print(f"LOCKDEP FAILED: {len(split['unwaived'])} unwaived "
              f"violation(s):")
        for v in split["unwaived"]:
            print(concurrency.format_violation(v))
        session.exitstatus = 1
